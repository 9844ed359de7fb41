"""Share of device self time under ``attn.swa``: the sliding layers' norm, projections and
rotary embedding, the in-place read of their window pages (decode), the band attention
(prefill) and the out projection.
"""

from perfbench.lib import scopes_swa

NAME = "attn.window_time_share.mellum"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.share_pct(run, ('attn.swa',))
