"""Share of device self time under ``attn.full``: the full-attention layers' norm,
projections and rotary embedding, the in-place read of their K|V pages (decode), the
prefill attention and the out projection.
"""

from perfbench.lib import families

NAME = "attn.full_time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct", ("attn.full",))
