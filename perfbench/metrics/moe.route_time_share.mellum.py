"""Share of device self time under ``moe.route``: the float32 router over 64 experts, the
softmax, the top-8, and the sort of the (token, choice) pairs by expert.
"""

from perfbench.lib import scopes_swa

NAME = "moe.route_time_share.mellum"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.share_pct(run, ('moe.route',))
