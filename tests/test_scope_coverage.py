"""Every op of the programs the cells run lies under a ``jax.named_scope``
of the package, so that a device trace names the time it shows
(``perfbench/lib/slicereaders.py`` ``scoped_time_share``).

Walked on the jaxprs of one tiny configuration a family
(``scripts/decode_jaxpr.py`` builds them): an equation's path is the name
stacks from the program down to it. What may stay outside a scope is index
and layout plumbing, by primitive: XLA folds it into its consumers."""

import os
import re
import sys

import jax
import pytest
from jax._src import core as jcore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from scripts import decode_jaxpr  # noqa: E402

PACKAGE = os.path.join(ROOT, "distributed_inference_engine_tpu")
# one engine a family, on the body a TPU runs where the CPU can trace it
ENGINES = ("mistral_int4_window", "mistral_int4_inline", "ling_tiny",
           "olmo_tiny_kernel", "xing_tiny_kernel", "mellum_tiny_kernel",
           "kimi_tiny_kernel", "keye_tiny_kernel")
# layout and index plumbing that needs no name
PLUMBING = {"reshape", "slice", "squeeze", "iota", "broadcast_in_dim"}
SCOPE = re.compile(r'named_scope\(f?"([^"]+)"\)')


def program_scopes():
    """The package's scope names, read from its source as text; the one
    template (``attn.{kind}``) as a pattern."""
    names = set()
    for d, _sub, files in os.walk(PACKAGE):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as src:
                    names.update(SCOPE.findall(src.read()))
    return re.compile("|".join(
        re.sub(r"\\\{\w+\\\}", r"\\w+", re.escape(n)) for n in sorted(names)))


def leaves(jaxpr, prefix=()):
    """``(path segments, equation)`` of every equation that holds no
    jaxpr, through scans, loops, calls and kernels."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        path = prefix + tuple(s for s in stack.split("/") if s)
        subs = list(jcore.jaxprs_in_params(eqn.params))
        for sub in subs:
            yield from leaves(sub, path)
        if not subs:
            yield path, eqn


@pytest.fixture(scope="module")
def engines():
    built = {}

    def get(name):
        if name not in built:
            built.update(decode_jaxpr.engines(only=(name,)))
        return built[name]

    return get


@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("name", ENGINES)
def test_every_op_of_a_cells_program_lies_under_a_named_scope(
        name, kind, engines):
    scopes = program_scopes()
    (fn, args), = [(f, a) for k, f, a in decode_jaxpr.programs(engines(name))
                   if k == kind]
    bare, total = [], 0
    for path, eqn in leaves(jax.make_jaxpr(fn)(*args).jaxpr):
        total += 1
        if eqn.primitive.name in PLUMBING:
            continue
        if not any(scopes.fullmatch(seg) for seg in path):
            bare.append(("/".join(path), eqn.primitive.name))
    assert total > 100
    assert not bare, bare[:20]


def test_no_new_scope_reads_as_a_programs_kind():
    """``perfbench/lib/scopes.py`` tells a decode program by ``decode`` in
    an op's path: only the two kernels' scopes, which lie inside those
    programs, may carry it, and none carries ``prefill`` outside one."""
    names = program_scopes().pattern.split("|")
    assert {n for n in names if "decode" in n} == {"flash_decode"}
    assert {n for n in names if "prefill" in n} == {
        "flash_prefill", re.escape("attn.gdn.prefill"),
        re.escape("attn.kda.prefill")}
