"""Share of device self time under ``moe.experts``: the gather of the HELD assignments' rows,
the two grouped products over the 12 held experts and the sum back to tokens, in row
blocks under a loop that ends with the held count (``ops/moe_routed.py`` ``moe_block_held``).
"""

from perfbench.lib import scopes_mla_share

NAME = "moe.experts_time_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.share_pct(run, ('moe.experts',))
