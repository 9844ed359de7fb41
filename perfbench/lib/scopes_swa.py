"""What the ``*.mellum`` readers need beyond ``lib/scopes.py`` (whose list of
scopes is fixed and gives an op to the FIRST scope on its path): device self
time under the sliding-window family's own scopes, each read alone, and the
shares of the chip's peaks its cell reports.

Same sources as ``lib/scopes.py`` and ``lib/scopes_gdn.py``:
``hostspans.scoped_ops`` reads each op's scope path from the ``.xplane.pb``,
``tracered.leaf_ops`` gives it its self time; the reduction is kept beside
the trace in a file of its own (``scopes-swa-<wid>.json``). A scope nested in
another (``flash_decode`` in ``attn.swa`` and in ``attn.full``, ``gmm`` in
``moe.experts``) is counted under both, and ``flash_decode`` once more under
the kind it ran for (``attn.swa/flash_decode``): each name's seconds are
read alone, none is a sum of others.

The shares of a peak divide the traced slice's device seconds by bytes that
have to be the SLICE's own, as ``lib/scopes_gdn.py`` says: the rows and the
experts a step come from ``counters.json`` (the engine's counters as they
stood when the trace began and when it was asked to end), and the steps from
the trace itself: every call of the FULL layers' attention kernel inside the
slice is counted, so a chunk that the slice's edge cuts counts for the steps
of it that ran, and no share reads over 100 % for that.

Every reader returns ``None`` when what it reads is not there: a program
without the scope, the counters or the stamps (an earlier commit, another
family) leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from . import scopes
from .scopes import least_seconds as _least_seconds, per_slice_step
from .tracered import leaf_ops

SCOPES = ("attn.swa", "attn.full", "flash_decode", "attn.kv_update",
          "moe.route", "moe.experts", "gmm", "head.unembed", "sample")
KINDS = ("attn.swa", "attn.full")
# a trace is this family's when its ops carry this one
OWN = "attn.swa"


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}},
    "kernel_calls", "kernel_s"}`` over the device planes' ops, for
    ``SCOPES`` and ``<kind>/flash_decode``; every scope on an op's path
    takes the op. The kernel counted is the ONE operation under
    ``attn.full/flash_decode`` in the decode programs that takes most of
    that scope's time: it runs once a full layer a step."""
    busy = 0.0
    out: Dict[str, Dict[str, float]] = {}
    under: Dict[str, List[float]] = {}          # path -> [calls, seconds]

    def add(name, kind, ns):
        d = out.setdefault(name, {"decode": 0.0, "other": 0.0})
        d[kind] += ns / 1e9

    for ops in scoped_ops:
        for path, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy += self_ns
            kind = "decode" if "decode" in path.lower() else "other"
            for name in SCOPES:
                if f"/{name}/" in path:
                    add(name, kind, self_ns)
            for k in KINDS:
                if f"/{k}/" in path and "/flash_decode/" in path:
                    add(f"{k}/flash_decode", kind, self_ns)
            if kind == "decode" and "/attn.full/" in path \
                    and "/flash_decode/" in path:
                c = under.setdefault(path, [0, 0.0])
                c[0] += 1
                c[1] += self_ns / 1e9
    calls, seconds = max(under.values(), key=lambda c: c[1], default=(0, 0.0))
    return {"busy_s": busy / 1e9, "scopes": out,
            "kernel_calls": calls, "kernel_s": seconds}


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` summed over the workers' traced slices; ``None``
    without a trace or when no op carries the family's own scope."""
    total = scopes.summed_reductions(run, "scopes-swa", reduce_scopes)
    return total if OWN in total["scopes"] and total["busy_s"] else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    return scopes.share_of(scope_seconds(run), names)


_counts = scopes.counts_with


def steps_in_slice(run) -> Optional[float]:
    """Decode steps whose attention ran inside the traced slice: the full
    layers' kernel's calls there over the full layers a step runs."""
    sc = scope_seconds(run)
    counts = _counts(run, "widths")
    if not sc or not sc["kernel_calls"] or counts is None:
        return None
    return sc["kernel_calls"] / counts.widths(run.config)["L_full"]


def decode_step_ms(run) -> Optional[float]:
    return scopes.step_ms(run, steps_in_slice(run))


def prefill_time_share_pct(run) -> Optional[float]:
    return scopes.prefill_share_pct(run, scope_seconds(run))


def table_live_share_pct(run, kind: str) -> Optional[float]:
    """K|V rows the decode steps attended to in a layer of ``kind`` (``full``:
    the context; ``window``: min(context, window)) over rows the program
    says its attention read for them (the kernel's own count of the pages
    it copied, plus the side window)."""
    live = scopes.counter(run, "attn", f"{kind}_context_rows")
    table = scopes.counter(run, "attn", f"{kind}_table_rows")
    if live is None or not table:
        return None
    return 100.0 * live / table


def window_pages_held_share_pct(run) -> Optional[float]:
    """Window pages the live slots held over the pages the same contexts
    hold where nothing is cut (the full layers' pool), both summed by the
    allocator over the window's decode dispatches: the allocator's saving."""
    held = scopes.counter(run, "kv", "window_pages_held_sum")
    uncut = scopes.counter(run, "kv", "window_pages_uncut_sum")
    if held is None or not uncut:
        return None
    return 100.0 * held / uncut


def _slice_rows(run) -> Optional[Dict[str, float]]:
    """The slice's own counts a decode step, between the worker's two
    stamps: experts touched, (token, choice) pairs, K|V rows attended to a
    layer of each kind."""
    out = {name: per_slice_step(run, *path) for name, path in (
        ("touched", ("moe", "experts_touched")),
        ("pairs", ("moe", "decode_assignments_held")),
        ("full", ("attn", "full_context_rows")),
        ("window", ("attn", "window_context_rows")))}
    return None if any(v is None for v in out.values()) else out


def decode_stream_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take for the slice's decode steps (the
    experts that got a row, every layer's attention matrices and router and
    the head once a step, the LIVE K|V rows of both kinds:
    ``counts/swa_moe.py`` ``decode_stream_cost``) over the decode programs'
    device time: the whole step's share of the HBM peak."""
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "decode_stream_cost")
    if not n or rows is None or counts is None:
        return None
    seconds = run.trace["program_s"].get("decode")
    if not seconds:
        return None
    w = counts.widths(run.config)
    cost = counts.decode_stream_cost(
        run.config, n, rows["touched"] * n, rows["pairs"] * n,
        rows["full"] * n, rows["window"] * n,
        rows["pairs"] * n / (w["k"] * w["L"]))
    return 100.0 * _least_seconds(run, cost) / seconds


def attn_decode_roofline_pct(run) -> Optional[float]:
    """Least time to read the LIVE K|V rows the decode steps attended to in
    the layers of both kinds (counters ``attn.full_context_rows`` and
    ``attn.window_context_rows``, never the tables) over the decode
    programs' self time under ``flash_decode`` (the kernel and nothing
    else: the projections are outside that scope, and outside the bytes)."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "attn_decode_cost")
    if not sc or not n or rows is None or counts is None:
        return None
    seconds = sc["scopes"].get("flash_decode", {}).get("decode")
    if not seconds:
        return None
    return 100.0 * _least_seconds(run, counts.attn_decode_cost(
        run.config, rows["full"] * n, rows["window"] * n)) / seconds


def expert_stream_roofline_pct(run, scope: str = "moe.experts"
                               ) -> Optional[float]:
    """Least time to read the experts that got a row (counter
    ``moe.experts_touched``: touched, never all held) and their rows, over
    the decode programs' self time under ``scope``."""
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


def full_table_live_share_pct(run) -> Optional[float]:
    """The full layers' table, by the name the folded reader asks for."""
    return table_live_share_pct(run, "full")
