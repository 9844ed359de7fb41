"""Share of device self time under the ``moe.route`` scope: sigmoid scores, the
top-k, the sort of (token, choice) pairs by expert.
"""

from perfbench.lib import scopes

NAME = "moe.route_time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes.share_pct(run, ('moe.route',))
