"""Served chains against the plain reference's logits, teacher-forced.

A child of ``run.py``'s traced run, started after the servers stopped (it
needs the chip)::

    python perfbench/reference/check.py <job.json>

``job.json``: ``{"config": <configuration file's content>, "weight_seed",
"cases": [{"label", "prompt", "tokens"}]}``. What belongs to the
configuration's architecture comes from its family's module
(``reference/<family>.py``, found by ``lib/families.py``): the float32
``logits``, the configuration keys that must agree with the program's
``ModelSpec``, and the served tree rebuilt from the seed the way the worker
built it (the weights are data, not the reference). Every served token must
be the reference's argmax or lie inside its numeric tie set.

The tolerance is ``scripts/chip_parity.py``'s, set from chip runs (PR 21,
mistral-7b, 19 served chains x 64 steps): 70-92 % of the served tokens are
the exact float32 argmax, the rest sit up to 3.9 % of max|logit| below it —
bf16 activations re-rounded through 32 blocks, on random-init logits so flat
that the reference's own top two are within 6 % of each other on two thirds
of the steps. 2**-3 leaves the worst seen a 2x margin; a token from a wrong
computation (a lower precision, a dropped term) lands ~100 % away and
collapses the strict share.
"""

from __future__ import annotations

import json
import os
import sys

TIE_FRACTION = 2.0 ** -3
MIN_STRICT_SHARE = 0.5


def judge(lg_seq, n_prompt: int, tokens, tie_fraction: float = TIE_FRACTION,
          min_strict_share: float = MIN_STRICT_SHARE) -> dict:
    """Compare one chain with reference logits ``lg_seq`` [T, V] (numpy)."""
    import numpy as np

    strict = ties = bad = 0
    worst = 0.0
    for i, tok in enumerate(tokens):
        lg = lg_seq[n_prompt - 1 + i]
        eps = tie_fraction * float(np.max(np.abs(lg)))
        gap = float(lg.max() - lg[tok])
        if int(lg.argmax()) == tok:
            strict += 1
        elif gap < eps:
            ties += 1
            worst = max(worst, gap / eps)
        else:
            bad += 1
    n = len(tokens)
    return {"strict": strict, "ties": ties, "outside": bad, "n": n,
            "worst_tie": worst,
            "ok": bad == 0 and strict >= min_strict_share * n}


def main(argv) -> int:
    with open(argv[0]) as f:
        job = json.load(f)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from distributed_inference_engine_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.models import spec_for_architecture
    from perfbench.lib import families

    cfg = job["config"]
    serve = cfg["serve"]
    ref = families.reference(cfg)
    tie = float(getattr(ref, "TIE_FRACTION", TIE_FRACTION))
    share = float(getattr(ref, "MIN_STRICT_SHARE", MIN_STRICT_SHARE))
    dev = jax.devices()[0]
    print(f"reference: platform={dev.platform} kind={dev.device_kind!r} "
          f"family={families.family_name(cfg)} tie_fraction={tie} "
          f"min_strict_share={share}", flush=True)
    spec = spec_for_architecture(serve["architecture"], size=serve["size"],
                                 max_seq_len=serve["max_seq_len"])
    for key, field in ref.SPEC_PAIRS:
        have = getattr(spec, field)
        if cfg[key] != have:
            print(f"reference: the program runs {key}={have}, the "
                  f"configuration file says {cfg[key]}", flush=True)
            return 1
    params = ref.build_params(cfg, spec, int(job["weight_seed"]))
    failed = 0
    for case in job["cases"]:
        seq = jnp.asarray(case["prompt"] + case["tokens"], jnp.int32)
        lg = np.asarray(ref.logits(cfg, params, seq))
        res = judge(lg, len(case["prompt"]), case["tokens"], tie, share)
        failed += not res["ok"]
        print(f"  {case['label']}: {json.dumps(res)}", flush=True)
    print(f"reference: {len(job['cases']) - failed}/{len(job['cases'])} "
          f"chains verified", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
