"""Share of device self time under the ``moe.experts`` scope: the grouped products
over the experts, their row gather and combine.
"""

from perfbench.lib import scopes

NAME = "moe.experts_time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes.share_pct(run, ('moe.experts',))
