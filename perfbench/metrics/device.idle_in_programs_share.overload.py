"""Idle time INSIDE programs over the slice's window: over the "XLA Modules" events, each
module's time less the union of the ops inside it.
"""

from perfbench.lib import slicereaders

NAME = "device.idle_in_programs_share.overload"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return slicereaders.share_pct(run, "idle_in_programs_s")
