"""Share of device self time under ``attn.full``: the full layers' norm, projections and
YaRN rotary embedding, the in-place read of their K|V pages (decode), the blocked causal
attention (prefill) and the out projection.
"""

from perfbench.lib import scopes_swa

NAME = "attn.full_time_share.mellum"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.share_pct(run, ('attn.full',))
