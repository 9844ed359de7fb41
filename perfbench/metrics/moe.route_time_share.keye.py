"""Share of device self time under ``moe.route``: the float32 router over 128 experts, its softmax
and top-8.
"""

from perfbench.lib import scopes_dsa

NAME = "moe.route_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('moe.route',))
