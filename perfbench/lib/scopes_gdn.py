"""What the ``*.olmo`` readers need beyond ``lib/scopes.py`` (whose list of
scopes is fixed: it names none of this family's): device self time under the
Gated-DeltaNet family's own scopes, and the three shares of the HBM peak its
cell reports.

Same sources as ``lib/scopes.py``: ``hostspans.scoped_ops`` reads each op's
scope path from the ``.xplane.pb``, ``tracered.leaf_ops`` gives it its self
time; the reduction is kept beside the trace in a file of its own
(``scopes-gdn-<wid>.json``). A scope nested in another (``recurrence`` in
``attn.gdn.step``, ``flash_decode`` in ``attn.full``) is counted under both:
each name's seconds are read alone, none is a sum of others.

The three shares of the peak divide the traced slice's device seconds by
bytes that have to be the SLICE's own (eight rows' contexts in one 4 s slice
lie up to a third off the window's mean). Two things make them so. The rows
a step come from ``counters.json``, which the worker writes into the trace
directory: the engine's counters as they stood when the trace began and when
it was asked to end (at most one chunk of ~19 after the programs they
count). The steps come from the trace itself: every call of the attention
kernel inside the slice is counted (``kernel_calls``), so a chunk that the
slice's edge cuts counts for the steps of it that ran (the trace gives it
its cut duration but ``program_calls`` a whole call: 16 steps of ~300).

Every reader returns ``None`` when what it reads is not there: a program
without the scope, the counters or the stamps (an earlier commit, another
family) leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from . import scopes
from .scopes import least_seconds as _least_seconds, per_slice_step
from .tracered import leaf_ops

SCOPES = ("attn.gdn.step", "attn.gdn.prefill", "recurrence", "attn.full",
          "flash_decode", "state.update", "mlp.dense", "head.unembed",
          "sample")
# a trace is this family's when its ops carry one of these
OWN = ("attn.gdn.step", "attn.gdn.prefill")


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}},
    "kernel_calls", "kernel_s"}`` over the device planes' ops, for
    ``SCOPES``; every scope on an op's path takes the op. The kernel is the
    ONE operation under ``flash_decode`` in the decode programs that takes
    most of that scope's time (the few small ops beside it prepare its
    scalars): how often it ran inside the slice, and for how long."""
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
            if kind == "decode" and "/flash_decode/" in path:
                c = under.setdefault(path, [0, 0.0])
                c[0] += 1
                c[1] += self_ns / 1e9
    calls, seconds = max(under.values(), key=lambda c: c[1], default=(0, 0.0))
    return {"busy_s": busy / 1e9, "scopes": out,
            "kernel_calls": calls, "kernel_s": seconds}


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` summed over the workers' traced slices; ``None``
    without a trace or when no op carries one of the family's own scopes."""
    total = scopes.summed_reductions(run, "scopes-gdn", reduce_scopes)
    own = any(n in total["scopes"] for n in OWN)
    return total if own and total["busy_s"] else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    return scopes.share_of(scope_seconds(run), names)


_counts = scopes.counts_with


def steps_in_slice(run) -> Optional[float]:
    """Decode steps whose attention ran inside the traced slice: the
    kernel's calls there over the full-attention layers a step runs."""
    sc = scope_seconds(run)
    counts = _counts(run, "widths")
    if not sc or not sc["kernel_calls"] or counts is None:
        return None
    return sc["kernel_calls"] / counts.widths(run.config)["L_full"]


def decode_step_ms(run) -> Optional[float]:
    return scopes.step_ms(run, steps_in_slice(run))


def prefill_time_share_pct(run) -> Optional[float]:
    return scopes.prefill_share_pct(run, scope_seconds(run))


def table_live_share_pct(run) -> Optional[float]:
    """K|V rows the decode steps attended to over rows the program says its
    attention read (the kernel's own count of the pages it copied)."""
    live = scopes.counter(run, "attn", "full_context_rows")
    table = scopes.counter(run, "attn", "full_table_rows")
    if live is None or not table:
        return None
    return 100.0 * live / table


def decode_stream_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take for the slice's decode steps (every
    kept weight and the head once a step, the LIVE K|V rows, the live states
    read and written: ``counts/gdn_hybrid.py`` ``decode_stream_cost``) over
    the decode programs' device time: the whole step's share of the HBM
    peak."""
    n = steps_in_slice(run)
    rows = per_slice_step(run, "attn", "full_context_rows")
    moved = per_slice_step(run, "state", "rows_updated")
    counts = _counts(run, "decode_stream_cost")
    if (not n or rows is None or moved is None or counts is None
            or scope_seconds(run) is None):
        return None
    seconds = run.trace["program_s"].get("decode")
    if not seconds:
        return None
    cost = counts.decode_stream_cost(run.config, n, rows * n, moved * n)
    return 100.0 * _least_seconds(run, cost) / seconds


def full_decode_roofline_pct(run) -> Optional[float]:
    """Least time to read the LIVE K|V rows the decode steps attended to
    (counter ``attn.full_context_rows``, never the table) over the decode
    programs' self time under ``flash_decode`` (the kernel and nothing
    else: its projections are outside that scope, and outside the bytes)."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    rows = per_slice_step(run, "attn", "full_context_rows")
    counts = _counts(run, "full_decode_cost")
    if not sc or not n or rows is None or counts is None:
        return None
    seconds = sc["scopes"].get("flash_decode", {}).get("decode")
    if not seconds:
        return None
    return 100.0 * _least_seconds(
        run, counts.full_decode_cost(run.config, rows * n)) / seconds


def state_roofline_pct(run) -> Optional[float]:
    """Least time to read and write the states the decode steps moved
    (counter ``state.rows_updated``: live rows, never all slots) over the
    decode programs' self time under ``recurrence`` and ``state.update``
    (the delta rule's step and the masked write-back; the projections and
    the convolution are outside both, and outside the bytes)."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    moved = per_slice_step(run, "state", "rows_updated")
    counts = _counts(run, "state_cost")
    if not sc or not n or moved is None or counts is None:
        return None
    seconds = sum(sc["scopes"].get(s, {}).get("decode", 0.0)
                  for s in ("recurrence", "state.update"))
    if not seconds:
        return None
    return 100.0 * _least_seconds(
        run, counts.state_cost(run.config, moved * n)) / seconds


# the names the folded readers ask every family's module for
full_table_live_share_pct = table_live_share_pct
