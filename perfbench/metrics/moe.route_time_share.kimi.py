"""Share of device self time under ``moe.route``: the float32 router over all 384 published
experts, the sigmoid, the top-8 of score + bias, and the sort of the (token, choice) pairs
that puts the held ones first.
"""

from perfbench.lib import scopes_mla_share

NAME = "moe.route_time_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.share_pct(run, ('moe.route',))
