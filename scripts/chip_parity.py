"""Teacher-forced check of greedy chains against single-device logits.

Child of ``chip_smoke.py``'s tp=4 leg, started after the servers exited (it
needs the chip). Two greedy chains from differently sharded engines cannot
be compared token by token: a near-tie may flip one chain, after which every
later token differs by construction (``utils/parity.py``). So each chain is
fed back through the single-device forward and EVERY step must be the
reference argmax or lie inside its numeric tie set.

    python -m scripts.chip_parity cases.json

``cases.json``: ``{"architecture", "size", "max_seq_len", "cases":
[{"label", "prompt", "tokens"}, ...]}``. The weights are rebuilt from the
deploy's seed exactly as ``models.engine_from_config`` builds them
(random-init packed int4, seed 0, bf16 embeddings and norms) and then
evaluated the plain way: float32 activations, no Pallas kernel (int4 kernel
mode "off": XLA dequant einsum), ``jax.default_matmul_precision("highest")``
— so the rounding noise in the comparison is the served path's alone.
"""

from __future__ import annotations

import json
import sys

# A candidate within this fraction of the row's largest |logit| of the
# reference max counts as tied. Set from chip runs (PR 21, mistral-7b, 19
# served chains x 64 steps against this reference — one chip, and tp=4 on the
# XLA int4 path): 70-92% of the steps pick the exact float32 argmax, the
# rest sit up to 3.9% (one chip) / 5.8% (tp=4) of max|logit| below it — bf16
# activations re-rounded through 32 blocks, on random-init logits so flat
# that the reference's own top-2 are within 6% of each other on two thirds
# of the steps. 2**-3 = 12.5% leaves the worst seen a 2x margin; a token
# from a wrong computation lands ~100% away and collapses the strict share.
TIE_FRACTION = 2.0 ** -3
MIN_STRICT_SHARE = 0.5      # a chain that is mostly "ties" proves nothing


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    with open(path) as f:
        job = json.load(f)

    from distributed_inference_engine_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_engine_tpu.models import spec_for_architecture
    from distributed_inference_engine_tpu.models.base import forward_train
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        set_kernel_mode,
    )
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    dev = jax.devices()[0]
    print(f"chip_parity: platform={dev.platform} "
          f"device_kind={dev.device_kind!r}", flush=True)
    spec = spec_for_architecture(job["architecture"], size=job["size"],
                                 max_seq_len=job["max_seq_len"])
    params = random_quantized_params(spec.replace(dtype="bfloat16"),
                                     jax.random.key(0), bits=4)
    # the served VALUES (bf16-rounded embeddings and norms, int4 payloads),
    # widened exactly to float32 for the plain evaluation
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)
    spec = spec.replace(dtype="float32")
    set_kernel_mode("off")
    cases = job["cases"]
    seqs = jnp.asarray([c["prompt"] + c["tokens"] for c in cases], jnp.int32)
    lens = jnp.full((seqs.shape[0],), seqs.shape[1], jnp.int32)

    @jax.jit
    def fwd(p, t, n):
        with jax.default_matmul_precision("highest"):
            return forward_train(spec, p, t, n)

    failed = 0
    # a few sequences per dispatch bounds the [B, T, V] fp32 logits
    for a in range(0, len(cases), 4):
        logits = np.asarray(fwd(params, seqs[a:a + 4], lens[a:a + 4]))
        for case, lg_seq in zip(cases[a:a + 4], logits):
            n_prompt = len(case["prompt"])
            strict = ties = close = 0
            worst = 0.0
            bad = []
            for i, tok in enumerate(case["tokens"]):
                lg = lg_seq[n_prompt - 1 + i]
                eps = TIE_FRACTION * float(np.max(np.abs(lg)))
                gap = float(lg.max() - lg[tok])
                top2 = np.partition(lg, -2)[-2:]
                close += float(top2[1] - top2[0]) < eps
                if int(lg.argmax()) == tok:
                    strict += 1
                elif gap < eps:
                    ties += 1
                    worst = max(worst, gap / eps)
                else:
                    bad.append((i, tok, int(lg.argmax()), gap / eps))
            n = len(case["tokens"])
            ok = not bad and strict >= MIN_STRICT_SHARE * n
            failed += not ok
            print(f"  {case['label']}: {strict}/{n} reference argmax, "
                  f"{ties} inside the tie set (worst at {worst:.2f} of its "
                  f"width; the reference's own top-2 are that close on "
                  f"{close} steps), {len(bad)} outside"
                  + (f" — first (step, token, argmax, gap/width): {bad[0]}"
                     if bad else ""), flush=True)
    print(f"chip_parity: {len(cases) - failed}/{len(cases)} chains verified",
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
