"""What the ``*.kimi`` readers need beyond ``lib/scopes.py`` (whose list of
scopes is fixed and gives an op to the FIRST scope on its path): device self
time under the scopes the ``mla_moe_share`` family's programs carry
(``attn.mla``, ``moe.route``, ``moe.experts``, ``gmm``, ``head.unembed``,
``sample``), each read alone, the latent decode kernel's own calls and
seconds, and the four shares of the chip's peaks its cell reports.

Same sources as ``lib/scopes.py`` and ``lib/scopes_gdn.py``:
``hostspans.scoped_ops`` reads each op's scope path from the ``.xplane.pb``,
``tracered.leaf_ops`` gives it its self time; the reduction is kept beside
the trace in a file of its own (``scopes-mla-share-<wid>.json``). A scope
nested in another (``gmm`` in ``moe.experts``) is counted under both: each
name's seconds are read alone, none is a sum of others.

The shares of a peak divide the traced slice's device seconds by bytes that
have to be the SLICE's own, as ``lib/scopes_gdn.py`` says (ROADMAP B1: the
window's means over the slice's seconds read 102.8 % on an unchanged
program): the rows and the experts a step come from ``counters.json`` (the
engine's counters as they stood when the trace began and when it was asked
to end), and the steps from the trace itself: every call of the latent
decode kernel inside the slice is counted, one a layer a step, so a chunk
that the slice's edge cuts counts for the steps of it that ran.

The kernel is found by its path: the jitted ``latent_decode_attention_pallas``
names every op it holds, and the ONE path under it in the decode programs
that takes most of that time is the Mosaic kernel (the few small ops beside
it prepare its scalars and cut its padded heads).

Every reader returns ``None`` when what it reads is not there: a program
without the scope, the kernel, the counters or the stamps (an earlier
commit, another family) leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from . import scopes
from .scopes import least_seconds as _least_seconds, per_slice_step
from .tracered import leaf_ops

SCOPES = ("attn.mla", "attn.kv_update", "moe.route", "moe.experts",
          "moe.shared", "gmm", "head.unembed", "sample")
KERNEL = "latent_decode"       # in the path of every op of the kernel's jit


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}},
    "kernel_calls", "kernel_s"}`` over the device planes' ops, for
    ``SCOPES``; every scope on an op's path takes the op."""
    busy = 0.0
    out: Dict[str, Dict[str, float]] = {}
    under: Dict[str, List[float]] = {}          # path -> [calls, seconds]
    for ops in scoped_ops:
        for path, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy += self_ns
            kind = "decode" if "decode" in path.lower() else "other"
            for name in SCOPES:
                if f"/{name}/" in path:
                    d = out.setdefault(name, {"decode": 0.0, "other": 0.0})
                    d[kind] += self_ns / 1e9
            if kind == "decode" and KERNEL in path:
                c = under.setdefault(path, [0, 0.0])
                c[0] += 1
                c[1] += self_ns / 1e9
    calls, seconds = max(under.values(), key=lambda c: c[1], default=(0, 0.0))
    return {"busy_s": busy / 1e9, "scopes": out,
            "kernel_calls": calls, "kernel_s": seconds}


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` summed over the workers' traced slices; ``None``
    without a trace or when no op ran under ``attn.mla``."""
    total = scopes.summed_reductions(run, "scopes-mla-share", reduce_scopes)
    return total if "attn.mla" in total["scopes"] and total["busy_s"] else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    return scopes.share_of(scope_seconds(run), names)


_counts = scopes.counts_with


def steps_in_slice(run) -> Optional[float]:
    """Decode steps whose attention ran inside the traced slice: the latent
    kernel's calls there over the layers a step runs (every one is MLA)."""
    sc = scope_seconds(run)
    counts = _counts(run, "widths")
    if not sc or not sc["kernel_calls"] or counts is None:
        return None
    return sc["kernel_calls"] / counts.widths(run.config)["L"]


def decode_step_ms(run) -> Optional[float]:
    return scopes.step_ms(run, steps_in_slice(run))


def prefill_time_share_pct(run) -> Optional[float]:
    return scopes.prefill_share_pct(run, scope_seconds(run))


def table_live_share_pct(run) -> Optional[float]:
    """Latent rows the decode steps attended to over rows the program says
    its attention read for them (the kernel's own count of the pages it
    copied x 128, plus the side window)."""
    live = scopes.counter(run, "mla", "decode_context_rows")
    table = scopes.counter(run, "mla", "decode_table_rows")
    if live is None or not table:
        return None
    return 100.0 * live / table


def held_assignment_share_pct(run) -> Optional[float]:
    """Top-k choices that landed on the experts this chip holds over all
    choices (prefill and decode), across the window."""
    held = scopes.counter(run, "moe", "assignments_held")
    total = scopes.counter(run, "moe", "assignments_total")
    return 100.0 * held / total if held is not None and total else None


def _slice_rows(run) -> Optional[Dict[str, float]]:
    """The slice's own counts a decode step, between the worker's two
    stamps: experts touched, HELD (token, choice) pairs, latent rows
    attended to a layer, live rows (a live row emits one token a step)."""
    out = {name: per_slice_step(run, *path) for name, path in (
        ("touched", ("moe", "experts_touched")),
        ("pairs", ("moe", "decode_assignments_held")),
        ("context", ("mla", "decode_context_rows")),
        ("live", ("total_generated_tokens",)))}
    return None if any(v is None for v in out.values()) else out


def decode_stream_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take for the slice's WHOLE decode steps
    (every kept weight outside the routed experts and the head once a
    step, the experts TOUCHED, the live latent rows:
    ``counts/mla_moe_share.py`` ``decode_stream_cost``) over the decode
    programs' device time."""
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "decode_stream_cost")
    if not n or rows is None or counts is None:
        return None
    seconds = run.trace["program_s"].get("decode")
    if not seconds:
        return None
    cost = counts.decode_stream_cost(
        run.config, n, rows["touched"] * n, rows["pairs"] * n,
        rows["context"] * n, rows["live"] * n)
    return 100.0 * _least_seconds(run, cost) / seconds


def mla_decode_roofline_pct(run) -> Optional[float]:
    """Least time for the latent kernel's work in the slice (the LIVE rows x
    1,152 B a layer at the HBM peak, or the absorbed products' 139 kFLOP a
    row a layer at the bf16 peak, whichever is larger:
    ``counts/mla_moe_share.py`` ``mla_decode_cost``) over the kernel's own
    seconds (its projections are outside, and outside the count)."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "mla_decode_cost")
    if not sc or not n or rows is None or counts is None:
        return None
    if not sc["kernel_s"]:
        return None
    return 100.0 * _least_seconds(run, counts.mla_decode_cost(
        run.config, rows["context"] * n)) / sc["kernel_s"]


def expert_stream_roofline_pct(run, scope: str = "moe.experts"
                               ) -> Optional[float]:
    """Least time to read the experts that got a row (counter
    ``moe.experts_touched``: touched, never all held) and their held rows,
    over the decode programs' self time under ``scope``."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "expert_stream_cost")
    if not sc or not n or rows is None or counts is None:
        return None
    seconds = sc["scopes"].get(scope, {}).get("decode")
    if not seconds:
        return None
    return 100.0 * _least_seconds(run, counts.expert_stream_cost(
        run.config, rows["touched"] * n, rows["pairs"] * n)) / seconds


# the name the folded reader asks every latent-row family's module for
mla_table_live_share_pct = table_live_share_pct
