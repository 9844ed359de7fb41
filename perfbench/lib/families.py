"""The one seam by which the harness finds what belongs to an architecture.

A configuration file names its family in the key ``family`` (absent = the
dense int4 decoder ``dense_int4``). By that name the harness finds two files,
which a later PR adds without touching any that exist:

``counts/<family>.py`` (no jax: the benchmark process imports it)
    ``param_bytes(cfg)``: stored bytes of the served tree, as cut by the
    configuration's ``reduced`` keys; ``kv_bytes_per_token(cfg)``: what a
    token adds to the cache, with ``CACHE`` saying in a line what the cache
    holds (a family whose cache does not grow by the token says so there and
    returns its per-token part); ``weight_matmuls(cfg)``: every weight matrix
    a forward pass multiplies by, ``(name, K, N, times per pass, dtype)`` as
    stored.

    Optionally ``SCOPE_READERS``: the module under ``lib/`` that holds the
    family's scope readers (its list of ``jax.named_scope`` names, how it
    counts a slice, which of ``counts``' costs it divides by). A reading that
    several families have has ONE entry in ``BENCHMARK.json`` and one file
    under ``metrics/``, which asks the run's own family's module for it by
    the function's name (``scope_reading``); a module without that function
    has no such reading, and the reader returns ``None``. The names in use:
    ``decode_step_ms``, ``prefill_time_share_pct``,
    ``decode_stream_roofline_pct``, ``share_pct(run, scopes)``,
    ``expert_stream_roofline_pct(run[, scope])``,
    ``held_assignment_share_pct``, ``mla_decode_roofline_pct``,
    ``mla_table_live_share_pct``, ``full_table_live_share_pct``.

``reference/<family>.py`` (jax: a child of the traced run imports it)
    ``logits(cfg, params, tokens)``: the plain float32 reference;
    ``SPEC_PAIRS``: (configuration key, ``ModelSpec`` field) pairs that must
    agree before anything is compared; ``build_params(cfg, spec, seed)``: the
    served tree rebuilt from ``--seed`` the way the worker built it. A family
    may set ``TIE_FRACTION`` / ``MIN_STRICT_SHARE`` of its own, with the
    reason written beside it; without them ``check.py``'s hold.
"""

from __future__ import annotations

import importlib
import importlib.util
import os
from types import ModuleType
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = "dense_int4"
COUNTS_API = ("param_bytes", "kv_bytes_per_token", "weight_matmuls", "CACHE")
REFERENCE_API = ("logits", "SPEC_PAIRS", "build_params")


def family_name(cfg: Dict[str, Any]) -> str:
    return str(cfg.get("family") or DEFAULT)


def _load(kind: str, cfg: Dict[str, Any], api) -> ModuleType:
    name = family_name(cfg)
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"family {name!r} has no perfbench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [a for a in api if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"perfbench/{kind}/{name}.py lacks {missing}")
    return mod


def counts(cfg: Dict[str, Any]) -> ModuleType:
    return _load("counts", cfg, COUNTS_API)


def reference(cfg: Dict[str, Any]) -> ModuleType:
    return _load("reference", cfg, REFERENCE_API)


def scopes(cfg: Dict[str, Any]) -> Optional[ModuleType]:
    """The family's scope readers (``counts/<family>.py`` ``SCOPE_READERS``
    names the module under ``lib/``); ``None`` for a family that names
    none."""
    name = getattr(counts(cfg), "SCOPE_READERS", None)
    return importlib.import_module(f"{__package__}.{name}") if name else None


def scope_reading(run, reading: str, *args: Any) -> Optional[float]:
    """``reading`` as the run's own family reads it; ``None`` where the
    family has no such reading."""
    fn = getattr(scopes(run.config), reading, None)
    return fn(run, *args) if fn else None


def int4_calls_per_pass(cfg: Dict[str, Any]) -> int:
    """Kernel calls of one forward pass over the family's int4 matrices; 0
    for a family that stores none (the int4 questions are then not asked)."""
    return sum(times for *_x, times, dtype in counts(cfg).weight_matmuls(cfg)
               if dtype == "int4")
