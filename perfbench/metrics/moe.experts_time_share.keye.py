"""Share of device self time under ``moe.experts``: the gather of the routed rows, the two grouped
products over the experts that got a row, the gates and the scatter back.
"""

from perfbench.lib import scopes_dsa

NAME = "moe.experts_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('moe.experts',))
