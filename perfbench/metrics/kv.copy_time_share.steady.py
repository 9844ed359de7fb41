"""Share of device self time under the model programs' attn.kv_gather and
attn.kv_update scopes (the dense K/V context sliced out, a step's rows written in).
"""

from perfbench.lib import spanreaders

NAME = "kv.copy_time_share.steady"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return spanreaders.kv_copy_share_pct(run)
