"""Long served chains at the ``keyevl2-longctx-overload`` cell's own lengths,
judged by ``reference/check.py``'s ``judge`` against this family's float32
reference, and the controls a chain can show. A builder's tool, run on the
chip in ONE process (it holds the chip: no worker, no coordinator):

    python3 perfbench/tools/longchain_keye.py [--prompt 8192,24576] \\
        [--out 64] [--seed 7] [--controls dense,topk_halved,...]

``run.py`` judges two chains of 48 + 24 tokens, which never reach the top-k
of 2,048 rows: they see the attention, the experts and the index cache's
plumbing and NOT the selection. This serves, for each ``--prompt`` length
(default 8,192 and 24,576: 4 and 12 top-ks, 64 and 192 pages), a prompt +
64 tokens (four decode chunks: index scores over cached and side keys, the
top-2,048 over both, the gather, the side window written back four times)
through ``ContinuousEngine`` twice (alone, and again while 7 other slots are
live), then computes the reference's logits for the chain's last positions
(``tools/longchain_mellum.py``'s flow with this family's defaults), and the
same with one named term wrong (``reference/dsa_moe.py`` ``CONTROLS``: no
selection above all, which is what ``correct``'s short chains cannot see)
and with the whole reference in bfloat16. It prints, per chain and reading,
the strict count and the worst gap as a share of max|logit|, and for a
long chain two WITNESSES held to nothing: the bfloat16 reference's own
greedy tokens against the float32 reference (``bfloat16_tokens``: what one
precision below does to a chain of this length) and the same with no
selection in both (``dense_bfloat16_tokens``: what of it is the selection's
edge); ``--chains N``
serves N chains of ``run.py``'s own 48 + 24 first. It says PASS when every
served chain is inside the family's limits (``TIE_FRACTION`` /
``MIN_STRICT_SHARE`` for the short chains, ``LONG_TIE_FRACTION`` /
``LONG_MIN_STRICT_SHARE`` for the long ones) and, on the long chains, every
control outside (the reference's ``LONG_NOT_SEPARATED`` readings and the short
chains' are printed and not held to that). ``--config keye-tiny --prompt
90 --out 32 --others 3`` rehearses the control flow on the CPU from
``perfbench/rehearse/`` (its ``max_seq_len`` is 128, its top-k 16). Its own
file and no wrapper of ``longchain_xing.py`` as Kimi's is: the accepted
tools' ``main``s read their own families' counters by key, serve one
length and keep no logits for the witnesses.
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
    ap.add_argument("--config", default="keye-vl-2.0-30b-a3b-pp1")
    ap.add_argument("--prompt", default="8192,24576",
                    help="long chains' prompt lengths, comma-separated")
    ap.add_argument("--out", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--others", type=int, default=7)
    ap.add_argument("--chains", type=int, default=0,
                    help="short chains (run.py's 48 + 24) served alone first")
    ap.add_argument("--chain-prompt", type=int, default=48)
    ap.add_argument("--chain-out", type=int, default=24)
    ap.add_argument("--q-block", type=int, default=128,
                    help="queries a block of the reference's attention")
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

    lengths = [int(n) for n in str(args.prompt).split(",")]

    def req(p, n, rid):
        return GenerationRequest(prompt=list(p), max_new_tokens=n,
                                 temperature=0.0, eos_id=-1, request_id=rid)

    # short chains first, each served alone: run.py's lengths, more of them
    cases = []
    for i in range(args.chains):
        p = prompt(args.chain_prompt)
        (res,) = engine.generate([req(p, args.chain_out, f"s{i}")])
        cases.append((f"short-{i}", p, [int(t) for t in res.tokens]))
    print(f"longchain: {args.chains} short chains served alone in "
          f"{time.monotonic() - t0:.0f}s", flush=True)
    long_cases = []
    for n in lengths:
        chain = prompt(n)
        t1 = time.monotonic()
        (alone,) = engine.generate([req(chain, args.out, f"alone-{n}")])
        # 7 others first (one row a prefill), long enough to outlive the
        # chain
        other_len = max(8, n // 4)
        for i in range(args.others):
            engine.submit(req(prompt(other_len), 4 * args.out, f"o{n}-{i}"))
        engine.submit(req(chain, args.out, f"among-{n}"))
        done = {r.request_id: r for r in engine.run_until_idle()}
        among = done[f"among-{n}"]
        m = engine.get_metrics()
        same = sum(a == b for a, b in zip(alone.tokens, among.tokens))
        print(f"longchain: {n} + {args.out} served alone and among "
              f"{args.others} others in {time.monotonic() - t1:.0f}s "
              f"({len(done)} finished, decode_steps {m['decode_steps']}, "
              f"context rows {m['attn']['full_context_rows']}, selected "
              f"{m['attn']['rows_selected']}, index keys read "
              f"{m['attn']['index_table_rows']}); the two agree on {same} "
              f"of {args.out} tokens", flush=True)
        long_cases += [(f"alone-{n}", chain, [int(t) for t in alone.tokens]),
                       (f"among-{n}", chain, [int(t) for t in among.tokens])]
        del done
    params = engine.params
    engine.kv.k_pages = engine.kv.state = None
    del engine
    # the reference in blocks of fewer queries on a long chain: a block's
    # float32 scores of 32 heads over 24,640 keys beside the 8.75 GB tree
    ref.Q_BLOCK = min(ref.Q_BLOCK, args.q_block)

    limits = (float(ref.TIE_FRACTION), float(ref.MIN_STRICT_SHARE))
    # the long chains are judged by the family's long-chain limits (their
    # readings are other: a selection computed from bfloat16 activations)
    long_limits = (float(ref.LONG_TIE_FRACTION),
                   float(ref.LONG_MIN_STRICT_SHARE))
    out = {"limits": limits, "long_limits": long_limits, "rows": []}
    ok = True
    summary = {}
    readings = readings_of(args.controls or ",".join(
        ref.CONTROLS + tuple(n for n in ref.NOT_SEPARATED
                             if n not in ref.CONTROLS)))
    judged = {}            # (prompt, tokens) -> its rows: among = alone

    def note(label, kind, name, lg, tokens, held, t1):
        nonlocal ok
        strict, worst = gaps(lg, tokens)
        verdict = check.judge(
            lg, 1, tokens, *(limits if kind == "short" else long_limits))
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
        if held and name == "reference":
            ok = ok and verdict["ok"]
        elif held and name not in ref.LONG_NOT_SEPARATED and kind == "long":
            # a short chain (72 rows) need not show a wrong model
            ok = ok and not verdict["ok"]

    for label, p, tokens in cases + long_cases:
        kind = "short" if label.startswith("short-") else "long"
        same = judged.get((id(p), tuple(tokens)))
        if same:
            # the same tokens served among the others: the same readings
            for row in [r for r in out["rows"] if r["chain"] == same]:
                agg = summary[(kind, row["against"])]
                agg[0].append(row["worst_gap"])
                agg[1].append(row["strict"] / row["n"])
                agg[2] += row["judge_ok"]
                out["rows"].append(dict(row, chain=label, seconds=0.0))
            print(f"  {label}: the tokens of {same}, its readings",
                  flush=True)
            continue
        judged[(id(p), tuple(tokens))] = label
        seq = jnp.asarray(p + tokens, jnp.int32)

        def logits_of(**kw):
            return np.asarray(ref.logits(cfg, params, seq,
                                         last=len(tokens) + 1, **kw),
                              np.float32)[:-1]

        kept = {}
        for name, kw in readings:
            t1 = time.monotonic()
            kept[name] = logits_of(**kw)
            note(label, kind, name, kept[name], tokens, True, t1)
        if kind == "short" or "bfloat16" not in kept:
            continue
        # the witnesses of the long chains' band, judged as a served chain
        # is and held to nothing: the bfloat16 reference's OWN greedy tokens
        # at the served contexts against the float32 reference (what one
        # precision below does to a chain of this length), and the same
        # with no selection in both (what of it the selection's edge is)
        t1 = time.monotonic()
        pairs = [("bfloat16_tokens", kept["reference"], kept["bfloat16"])]
        if "dense" in kept:
            pairs.append(("dense_bfloat16_tokens", kept["dense"], logits_of(
                dtype=jnp.bfloat16, control="dense")))
        for name, exact, lower in pairs:
            note(label, kind, name, exact,
                 [int(t) for t in lower.argmax(-1)], False, t1)
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
    with open(os.path.join(ROOT, "chiprun_out",
                           f"longchain_keye-{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(f"longchain: {'PASS' if ok else 'FAIL'}: every served chain "
          f"{'is' if ok else 'is NOT'} inside the limits {limits} (long "
          f"chains: {long_limits}) with the controls outside on every long "
          f"chain (not held: {ref.LONG_NOT_SEPARATED})", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
