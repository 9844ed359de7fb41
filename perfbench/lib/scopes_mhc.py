"""The mHC family's scope readers (``counts/xing4_mhc.py``
``SCOPE_READERS``): ``lib/scopes.py``'s own for the scopes both per-layer
families carry (``attn.mla``, ``moe.route``, ``moe.experts``, ``gmm``,
``head.unembed``: taken over by name below), and beyond them device self
time under the residual path's own scope ``resid.mhc``, the prefill
programs' share of the device's time, and the latent attention's share of
its HBM bound in decode.

Same sources as ``lib/scopes.py``: ``hostspans.scoped_ops`` reads each op's
scope path from the ``.xplane.pb``, ``tracered.leaf_ops`` gives it its self
time; the reduction is kept beside the trace in a file of its own
(``scopes-mhc-<wid>.json``). Every reader returns ``None`` when what it reads
is not there: a program without the scope or the counters (an earlier
commit, another family) leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from . import families, scopes
from .scopes import (  # noqa: F401  (the family's readers by one name)
    decode_step_ms, expert_stream_roofline_pct, share_pct,
)
from .tracered import leaf_ops

SCOPES = ("resid.mhc",)
# what ``share_pct`` (``lib/scopes.py``'s) finds in this family's programs
SHARE_SCOPES = ("attn.mla", "moe.route", "moe.experts", "moe.shared",
                "head.unembed", "sample")


def reduce_scopes(scoped_ops: Sequence[Sequence[Sequence[Any]]]
                  ) -> Dict[str, Any]:
    """``{"busy_s", "scopes": {name: {"decode": s, "other": s}}}`` over the
    device planes' ops, for ``SCOPES``."""
    busy = 0.0
    out: Dict[str, Dict[str, float]] = {}
    for ops in scoped_ops:
        for path, _end, self_ns in leaf_ops([tuple(e) for e in ops]):
            busy += self_ns
            name = next((s for s in SCOPES if f"/{s}/" in path), None)
            if name is None:
                continue
            kind = "decode" if "decode" in path.lower() else "other"
            d = out.setdefault(name, {"decode": 0.0, "other": 0.0})
            d[kind] += self_ns / 1e9
    return {"busy_s": busy / 1e9, "scopes": out}


def scope_seconds(run) -> Optional[Dict[str, Any]]:
    """The family's reduction over the workers' traced slices; ``None``
    without a trace or when it is another program's."""
    total = scopes.summed_reductions(run, "scopes-mhc", reduce_scopes)
    return total if total["scopes"] and total["busy_s"] else None


def mhc_share_pct(run) -> Optional[float]:
    """Device self time under ``resid.mhc`` over all device self time."""
    sc = scope_seconds(run)
    if not sc:
        return None
    return 100.0 * sum(sc["scopes"].get("resid.mhc", {}).values()) \
        / sc["busy_s"]


def mhc_ms_per_decode_step(run) -> Optional[float]:
    sc = scope_seconds(run)
    n = scopes.decode_steps_in_slice(run)
    if not sc or not n:
        return None
    return 1e3 * sc["scopes"].get("resid.mhc", {}).get("decode", 0.0) / n


def prefill_time_share_pct(run) -> Optional[float]:
    return scopes.prefill_share_pct(run, scope_seconds(run))


def mla_table_live_share_pct(run) -> Optional[float]:
    """Rows the decode steps attended to over rows the body read for them."""
    live = scopes.counter(run, "mla", "decode_context_rows")
    table = scopes.counter(run, "mla", "decode_table_rows")
    if live is None or not table:
        return None
    return 100.0 * live / table


def mla_decode_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take to read the latent rows the decode
    steps attended to (the LIVE rows, counter ``mla.decode_context_rows``
    between the slice's two stamps, never the table) over the decode
    programs' self time under
    ``attn.mla``. Only what that scope surely reads is counted: the
    layers' MLA matrices are left out of the bytes (part of their read is
    charged to ops outside the scope), so the share is a floor of the
    attention's use of the HBM peak and cannot pass 100 %."""
    sc = scopes.scope_seconds(run)
    rows = scopes.slice_counter(run, "mla", "decode_context_rows")
    if not sc or rows is None:
        return None
    seconds = sc["scopes"].get("attn.mla", {}).get("decode")
    counts = families.counts(run.config)
    if not seconds or not hasattr(counts, "mla_decode_cost"):
        return None
    return 100.0 * scopes.least_seconds(
        run, counts.mla_decode_cost(run.config, rows)) / seconds
