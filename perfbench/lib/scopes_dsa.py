"""What the ``*.keye`` readers need beyond ``lib/scopes.py`` (whose list of
scopes is fixed and gives an op to the FIRST scope on its path): device self
time under the learned-sparse-attention family's own scopes, each read
alone, and the shares of the chip's peaks its cell reports.

Same sources as ``lib/scopes_swa.py``: ``hostspans.scoped_ops`` reads each
op's scope path from the ``.xplane.pb``, ``tracered.leaf_ops`` gives it its
self time; the reduction is kept beside the trace in a file of its own
(``scopes-dsa-<wid>.json``). A scope nested in another (``attn.index`` in
``attn.dsa``, ``gmm`` in ``moe.experts``) is counted under both: each name's
seconds are read alone, none is a sum of others; every name is kept apart
for the decode programs and for the others (the prefills).

The shares of a peak divide the traced slice's device seconds by bytes or
operations that have to be the SLICE's own, as ``lib/scopes_gdn.py`` says:
the rows, the pairs and the experts come from ``counters.json`` (the
engine's counters as they stood when the trace began and when it was asked
to end), and the steps from the trace itself: the runs, in the decode
programs inside the slice, of the head's product (the longest operation
under ``head.unembed``: once a step), so a chunk that the slice's edge cuts
counts for the steps of it that ran, and no share reads over 100 % for that.
(Not an operation of the selection: on a v5e ``lax.top_k``'s longest runs
twice a call, the row gather's three times and the attention's longest is a
``concatenate`` that runs twice; the first traced runs read 176 % of a peak
for counting them once a layer a step, my chip runs, PR 45, calls A and B.)

Every reader returns ``None`` when what it reads is not there: a program
without the scope, the counters or the stamps (an earlier commit, another
family) leaves the metric out.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence

from . import scopes
from .scopes import least_seconds as _least_seconds, per_slice_step
from .tracered import leaf_ops

SCOPES = ("attn.dsa", "attn.index", "attn.select", "attn.gather",
          "attn.sparse", "attn.kv_update", "moe.route", "moe.experts", "gmm",
          "head.unembed", "sample")
# a trace is this family's when its ops carry this one
OWN = "attn.select"
# the scope whose longest decode operation runs once a STEP
ONCE = "head.unembed"
# the prefill's two Mosaic kernels say a run's shape in their names
# (``ops/sparse_index.py``): rows, queries, keys
KERNEL = re.compile(
    r"/(index_scores_flash|sparse_prefill_flash)_b(\d+)q(\d+)k(\d+)/")


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}}, "steps",
    "kernels"}`` over the device planes' ops, for ``SCOPES``; every scope on
    an op's path takes the op. ``steps`` = the runs of the ONE decode
    operation under ``head.unembed`` that takes most of that scope's time
    (the head's product: once a step). ``kernels`` = ``{"<kernel>
    <rows> <queries> <keys>": [runs, seconds]}`` of the prefill's two
    kernels, by the shape their names give."""
    busy = 0.0
    out: Dict[str, Dict[str, float]] = {}
    under: Dict[str, List[float]] = {}          # path -> [calls, seconds]
    kernels: Dict[str, List[float]] = {}
    for ops in scoped_ops:
        for path, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy += self_ns
            kind = "decode" if "decode" in path.lower() else "other"
            for name in SCOPES:
                if f"/{name}/" in path:
                    d = out.setdefault(name, {"decode": 0.0, "other": 0.0})
                    d[kind] += self_ns / 1e9
            if kind == "decode" and f"/{ONCE}/" in path:
                c = under.setdefault(path, [0, 0.0])
                c[0] += 1
                c[1] += self_ns / 1e9
            m = KERNEL.search(path)
            if m:
                c = kernels.setdefault(" ".join(m.groups()), [0, 0.0])
                c[0] += 1
                c[1] += self_ns / 1e9
    steps, _s = max(under.values(), key=lambda c: c[1], default=(0, 0.0))
    return {"busy_s": busy / 1e9, "scopes": out, "steps": steps,
            "kernels": kernels}


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` summed over the workers' traced slices; ``None``
    without a trace or when no op carries the family's own scope."""
    total = scopes.summed_reductions(run, "scopes-dsa", reduce_scopes)
    return total if OWN in total["scopes"] and total["busy_s"] else None


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    return scopes.share_of(scope_seconds(run), names)


_counts = scopes.counts_with


def steps_in_slice(run) -> Optional[float]:
    """Decode steps that ran inside the traced slice: the head's runs."""
    sc = scope_seconds(run)
    return float(sc["steps"]) if sc and sc["steps"] else None


def decode_step_ms(run) -> Optional[float]:
    return scopes.step_ms(run, steps_in_slice(run))


def prefill_time_share_pct(run) -> Optional[float]:
    return scopes.prefill_share_pct(run, scope_seconds(run))


def ratio_pct(run, above: str, below: str) -> Optional[float]:
    """One counter of group ``attn`` over another, across the window."""
    a = scopes.counter(run, "attn", above)
    b = scopes.counter(run, "attn", below)
    if a is None or not b:
        return None
    return 100.0 * a / b


def _slice_rows(run) -> Optional[Dict[str, float]]:
    """The slice's own counts a decode step, between the worker's two
    stamps: experts touched, (token, choice) pairs, index keys scored (live)
    and read (the table), K|V rows selected, a layer."""
    out = {name: per_slice_step(run, *path) for name, path in (
        ("touched", ("moe", "experts_touched")),
        ("pairs", ("moe", "decode_assignments_held")),
        ("scored", ("attn", "index_rows_scored")),
        ("table", ("attn", "index_table_rows")),
        ("selected", ("attn", "rows_selected")))}
    return None if any(v is None for v in out.values()) else out


def decode_stream_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take for the slice's decode steps (the
    experts that got a row, every layer's attention and indexer matrices and
    router and the head once a step, the LIVE index keys and the K|V rows
    selected: ``counts/dsa_moe.py`` ``decode_stream_cost``) over the decode
    programs' device time: the whole step's share of the HBM peak."""
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
        rows["scored"] * n, rows["selected"] * n,
        rows["pairs"] * n / (w["k"] * w["L"]))
    return 100.0 * _least_seconds(run, cost) / seconds


def sparse_decode_roofline_pct(run) -> Optional[float]:
    """Least time to read the index keys the decode steps READ (the table's
    rows, padding included: what this body has to move) and the K|V rows
    they gathered (``counts/dsa_moe.py`` ``sparse_decode_cost``) over the
    decode programs' self time under ``attn.index``, ``attn.select``,
    ``attn.gather`` and ``attn.sparse``."""
    sc = scope_seconds(run)
    n = steps_in_slice(run)
    rows = _slice_rows(run)
    counts = _counts(run, "sparse_decode_cost")
    if not sc or not n or rows is None or counts is None:
        return None
    seconds = sum(sc["scopes"].get(s, {}).get("decode", 0.0) for s in (
        "attn.index", "attn.select", "attn.gather", "attn.sparse"))
    if not seconds:
        return None
    return 100.0 * _least_seconds(run, counts.sparse_decode_cost(
        run.config, rows["table"] * n, rows["selected"] * n)) / seconds


def _kernel_runs(run, kernel: str):
    """``[(rows, queries, keys, runs, seconds)]`` of one of the prefill's
    kernels inside the slice, by the shape its name gives; ``None`` where
    the trace is not this family's or its reduction counted no kernels."""
    sc = scope_seconds(run)
    if not sc or "kernels" not in sc:
        return None
    out = []
    for shape, (runs, seconds) in sc["kernels"].items():
        name, *dims = shape.split()
        if name == kernel:
            out.append((*map(int, dims), runs, seconds))
    return out


def _kernel_roofline_pct(run, kernel: str, cost: str, pairs_of
                         ) -> Optional[float]:
    """Least time for the pairs ``pairs_of(counts, rows, queries, keys,
    runs)`` gives each shape of ``kernel``'s runs in the slice, by the
    family's ``cost`` function, over the kernel's own self time; 0 where
    the slice holds this family's programs and no run of the kernel (no
    prompt above the top-k was prefilled in it)."""
    runs = _kernel_runs(run, kernel)
    counts = _counts(run, cost)
    if runs is None or counts is None:
        return None
    seconds = sum(r[-1] for r in runs)
    if not seconds:
        return 0.0
    pairs = sum(pairs_of(counts, b, q, k, n) for b, q, k, n, _s in runs)
    return 100.0 * _least_seconds(
        run, getattr(counts, cost)(run.config, pairs)) / seconds


def index_prefill_roofline_pct(run) -> Optional[float]:
    """The index-score kernel alone, from what RAN in the slice: its runs'
    (query, key) pairs, which their names give (every pair of a block of
    queries against the row's every key is scored), x heads x width x 2
    (``counts/dsa_moe.py`` ``index_kernel_cost``) at the bf16 peak over the
    kernel's own self time. Nothing of it is the host's count: a prompt
    admitted at a slice's edge is in for the runs of it that ran."""
    return _kernel_roofline_pct(
        run, "index_scores_flash", "index_kernel_cost",
        lambda _c, b, q, k, n: float(b * q * k * n))


def sparse_prefill_roofline_pct(run) -> Optional[float]:
    """The masked prefill kernel alone, a FLOOR: the fewest pairs its runs
    in the slice can have computed (a run's name gives its chunk and its
    bucket, nothing gives the prompt's length: ``counts/dsa_moe.py``
    ``sparse_prefill_least_pairs``) x 32 heads x 128 x 4 at the bf16 peak
    over the kernel's own self time."""
    return _kernel_roofline_pct(
        run, "sparse_prefill_flash", "sparse_prefill_kernel_cost",
        lambda c, b, q, k, n: c.sparse_prefill_least_pairs(
            run.config, b, q, k, n))


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
