"""Share of device self time under the model programs' attn.kv_gather and
attn.kv_update scopes (a layer's side rows read and written each step, a chunk's side rows written back).
"""

from perfbench.lib import spanreaders

NAME = "kv.copy_time_share.olmo"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.kv_copy_share_pct(run)
