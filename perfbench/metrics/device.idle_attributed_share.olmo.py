"""Share of device idle-gap time (gaps of at least MIN_GAP_NS) whose midpoint lies
under a named program span: the tracing's own coverage.
"""

from perfbench.lib import spanreaders

NAME = "device.idle_attributed_share.olmo"
LAYER = "device"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.idle_attributed_share_pct(run)
