"""The programs the benchmark's cells run, as text: ``str(jax.make_jaxpr)`` of
a ``ContinuousEngine``'s ``_decode_chunk`` (8 steps) and ``_prefill_pages``
for the specs of the cells at test size: mistral-tiny int4 (no sliding
window) on the ``window`` body (``pallas-decode_interpret``) and on ``dense``
(``xla``) and, since PR 44, with a sliding window of 64 on ``inline``,
``ling-tiny`` and, since PR 38, ``olmo-hybrid-tiny`` on the kernel
and on XLA (``hybrid``; a parent dumped before has no such files: ``compare``
walks its first directory's); since PR 40 ``xing-tiny`` and ``mellum-tiny``
(window 32) the same way; since PR 41 ``kimi-tiny`` (12 slots: the plain
residual and the held-rows expert layer); since PR 45 ``keye-tiny`` (a top-k
of 16) on XLA and, since PR 47, on the selection's three decode kernels, where
the package has them. To dump a parent that lacks an
entry, run THIS file over its package (``PYTHONPATH=<parent> python <this
file> dump <dir>``).

    JAX_PLATFORMS=cpu python -m scripts.decode_jaxpr dump <dir>
    python -m scripts.decode_jaxpr compare <dir-a> <dir-b> [-v]

A PR that must not change those programs dumps in a copy of its parent
(``git archive``) and in its own tree, then compares: equal files mean equal
programs, without the chip. ``compare`` drops equations whose only output is
``_`` (traced, read by nothing: XLA removes them) from both sides and says how
many each held. Of two files that differ it counts the equations that differ
by more than the NAMES of variables (an operand fewer renames every variable
after it and moves the line breaks: the names are struck out of both sides
and each equation laid on one line first), and ``-v`` prints them.
"""

from __future__ import annotations

import difflib
import os
import re
import sys

_DEAD = re.compile(r"^\s*_:[^\n]* = \w+\[\n(?:[^\n\]]*\n)*?\s*\] \w+\n", re.M)
# a variable: one to four letters, not where a primitive stands (after
# "= "), not a type (after ":", "<", "{") and not a parameter's key
_NAME = re.compile(r"(?<!= )(?<![\w.:<{])[a-z]{1,4}(?![\w=(.])")
_EQUATION = re.compile(r" (?=(?:%:\S+ )+= |\{ lambda|in \()")


def _equations(text: str):
    """The text with variables' names struck out, an equation a line."""
    flat = re.sub(r"\s+", " ", _NAME.sub("%", text))
    return _EQUATION.sub("\n", flat).splitlines()


def programs(eng):
    """``(kind, function, arguments)`` of the engine's decode chunk (8
    steps) and of one prefill (2 rows of a 32-token bucket), as
    ``jax.make_jaxpr`` takes them."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_engine_tpu.ops.sampling import SamplingParams

    kv, n = eng.kv, eng.max_slots
    sampling = SamplingParams(eng._temps, eng._top_k, eng._top_p, eng._min_p)
    key = jax.random.key(0)
    pages = 2 if eng.get_metrics()["attn_impl"] == "xla" else 0

    def decode(*args):
        return eng._decode_chunk(*args, n_steps=8, n_ctx_pages=pages,
                                 use_stops=True)

    # a package before PR 44's second commit passes a buffer of first
    # tokens through the chunk
    firsts = [eng._firsts_dev] if hasattr(eng, "_firsts_dev") else []
    yield "decode", decode, [
        eng.params, *kv.pools, eng._lengths, eng._last, eng._active,
        eng._produced, kv.page_table, jnp.zeros((n,), jnp.int32),
        eng._max_new, sampling, eng._eos, eng._stops_dev, *firsts, key]
    bb, tb = 2, 32
    args = [eng.params, jnp.zeros((bb, tb), jnp.int32),
            jnp.ones((bb,), jnp.int32), *kv.pools,
            jnp.zeros((bb, kv.max_pages_per_seq), jnp.int32),
            SamplingParams(jnp.zeros((bb,)), jnp.zeros((bb,), jnp.int32),
                           jnp.ones((bb,)), jnp.zeros((bb,))), key]
    if eng.spec.layer_kinds:
        args.append(jnp.zeros((bb,), jnp.int32))       # slot ids
    yield "prefill", lambda *a: eng._prefill_pages(*a), args


def _dump_engine(out: str, name: str, eng) -> None:
    import jax

    for kind, fn, args in programs(eng):
        with open(os.path.join(out, f"{name}.{kind}.txt"), "w") as f:
            f.write(str(jax.make_jaxpr(fn)(*args)))


def engines(only=None):
    """``(name, engine)`` of every entry (of those named in ``only``),
    built one at a time."""
    import jax

    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.models import (
        ling_spec,
        mistral_spec,
    )
    from distributed_inference_engine_tpu.models.mellum import mellum_spec
    from distributed_inference_engine_tpu.models.olmo_hybrid import (
        olmo_hybrid_spec,
    )
    from distributed_inference_engine_tpu.models import xing
    from distributed_inference_engine_tpu.models.xing import xing_spec
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    def skip(name):
        return only is not None and name not in only

    spec = mistral_spec("mistral-tiny", sliding_window=0, max_seq_len=128)
    params = random_quantized_params(spec, jax.random.key(0), bits=4)
    for name, impl, window in (
            ("mistral_int4_window", "pallas-decode_interpret", 0),
            ("mistral_int4_dense", "xla", 0),
            ("mistral_int4_inline", "auto", 64)):
        cfg = EngineConfig(max_slots=8, max_seq_len=128, page_size=16,
                           num_pages=72, prefill_buckets=[32, 64, 96],
                           decode_steps_per_call=8, attention_impl=impl)
        if skip(name):
            continue
        yield name, ContinuousEngine(
            spec.replace(sliding_window=window), params=params, config=cfg)
    cfg = EngineConfig(max_slots=4, max_seq_len=128, page_size=16,
                       num_pages=40, prefill_buckets=[32, 64],
                       decode_steps_per_call=8)
    if not skip("ling_tiny"):
        yield "ling_tiny", ContinuousEngine(ling_spec("ling-tiny"),
                                            config=cfg)
    for name, impl in (("olmo_tiny_kernel", "pallas-decode_interpret"),
                       ("olmo_tiny_xla", "xla")):
        cfg = EngineConfig(max_slots=4, max_seq_len=128, page_size=16,
                           num_pages=40, prefill_buckets=[32, 64],
                           decode_steps_per_call=8, attention_impl=impl)
        if not skip(name):
            yield name, ContinuousEngine(
                olmo_hybrid_spec("olmo-hybrid-tiny"), config=cfg)
        if not skip(name.replace("olmo", "xing")):
            yield name.replace("olmo", "xing"), ContinuousEngine(
                xing_spec("xing-tiny", max_seq_len=128), config=cfg)
        cfg = EngineConfig(max_slots=4, max_seq_len=256, page_size=8,
                           num_pages=128, prefill_buckets=[32, 64],
                           decode_steps_per_call=8, attention_impl=impl)
        if not skip(name.replace("olmo", "mellum")):
            yield name.replace("olmo", "mellum"), ContinuousEngine(
                mellum_spec("mellum-tiny", max_seq_len=256), config=cfg)
        # (a parent before PR 41 has no such spec)
        if not hasattr(xing, "kimi_spec") or skip(
                name.replace("olmo", "kimi")):
            continue
        cfg = EngineConfig(max_slots=12, max_seq_len=128, page_size=16,
                           num_pages=96, prefill_buckets=[32, 64],
                           decode_steps_per_call=8, attention_impl=impl)
        yield name.replace("olmo", "kimi"), ContinuousEngine(
            xing.kimi_spec("kimi-tiny", max_seq_len=128), config=cfg)
    try:
        from distributed_inference_engine_tpu.models.keye import keye_spec
    except ImportError:                         # a parent before PR 45
        return
    for name, impl in (("keye_tiny_xla", "xla"),
                       ("keye_tiny_kernel", "pallas-decode_interpret")):
        cfg = EngineConfig(max_slots=4, max_seq_len=256, page_size=8,
                           num_pages=128, prefill_buckets=[32, 64],
                           decode_steps_per_call=8, attention_impl=impl)
        if skip(name):
            continue
        try:
            engine = ContinuousEngine(
                keye_spec("keye-tiny", max_seq_len=256), config=cfg)
        except ValueError:      # a parent before PR 47: one body, XLA
            continue
        yield name, engine


def dump(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    for name, eng in engines():
        _dump_engine(out, name, eng)


def compare(a: str, b: str, verbose: bool = False) -> bool:
    same = True
    for name in sorted(os.listdir(a)):
        with open(os.path.join(a, name)) as f:
            ta = f.read()
        with open(os.path.join(b, name)) as f:
            tb = f.read()
        equal = _DEAD.sub("", ta) == _DEAD.sub("", tb)
        same &= equal
        print(f"{name}: byte_equal={ta == tb} equal_without_dead={equal} "
              f"dead_equations a={len(_DEAD.findall(ta))} "
              f"b={len(_DEAD.findall(tb))} chars={len(tb)}")
        if not equal:
            lines = [d for d in difflib.unified_diff(
                _equations(ta), _equations(tb), lineterm="", n=0)
                if d[0] in "+-" and d[:3] not in ("+++", "---")]
            print(f"  equations_differing_beyond_names={len(lines)}")
            if verbose:
                print("\n".join("    " + d for d in lines))
    return same


def main(argv) -> int:
    if len(argv) == 2 and argv[0] == "dump":
        dump(argv[1])
        return 0
    if len(argv) in (3, 4) and argv[0] == "compare" and argv[3:] in (
            [], ["-v"]):
        return 0 if compare(argv[1], argv[2], bool(argv[3:])) else 1
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
