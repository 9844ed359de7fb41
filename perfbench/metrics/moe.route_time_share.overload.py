"""Share of device self time under the ``moe.route`` scope: the router's product, its
scoring function, the top-k and the weights' normalisation.
"""

from perfbench.lib import families

NAME = "moe.route_time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct", ("moe.route",))
