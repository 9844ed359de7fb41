"""Share of device self time under the ``moe.experts`` scope: dispatch, the grouped matmuls
over the experts that got a row, and the combine.
"""

from perfbench.lib import families

NAME = "moe.experts_time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct", ("moe.experts",))
